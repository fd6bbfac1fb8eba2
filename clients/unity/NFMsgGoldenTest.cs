// GENERATED golden-vector replay harness - do not edit.
// Usage: NFMsgGoldenTest <path-to-NFMsgGolden.tsv>
// Compile next to the generated NFMsg.cs.

using System;
using System.IO;

public static class NFMsgGoldenTest
{
    static byte[] Roundtrip(string name, byte[] raw)
    {
        switch (name)
        {
            case "Ident": { var m = new NFMsg.Ident(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "Vector2": { var m = new NFMsg.Vector2(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "Vector3": { var m = new NFMsg.Vector3(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "MsgBase": { var m = new NFMsg.MsgBase(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "Position": { var m = new NFMsg.Position(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "PropertyInt": { var m = new NFMsg.PropertyInt(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "PropertyFloat": { var m = new NFMsg.PropertyFloat(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "PropertyString": { var m = new NFMsg.PropertyString(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "PropertyObject": { var m = new NFMsg.PropertyObject(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "PropertyVector2": { var m = new NFMsg.PropertyVector2(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "PropertyVector3": { var m = new NFMsg.PropertyVector3(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "ObjectPropertyList": { var m = new NFMsg.ObjectPropertyList(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "ObjectPropertyInt": { var m = new NFMsg.ObjectPropertyInt(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "ObjectPropertyFloat": { var m = new NFMsg.ObjectPropertyFloat(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "ObjectPropertyString": { var m = new NFMsg.ObjectPropertyString(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "ObjectPropertyObject": { var m = new NFMsg.ObjectPropertyObject(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "ObjectPropertyVector2": { var m = new NFMsg.ObjectPropertyVector2(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "ObjectPropertyVector3": { var m = new NFMsg.ObjectPropertyVector3(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "RecordInt": { var m = new NFMsg.RecordInt(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "RecordFloat": { var m = new NFMsg.RecordFloat(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "RecordString": { var m = new NFMsg.RecordString(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "RecordObject": { var m = new NFMsg.RecordObject(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "RecordVector2": { var m = new NFMsg.RecordVector2(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "RecordVector3": { var m = new NFMsg.RecordVector3(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "RecordAddRowStruct": { var m = new NFMsg.RecordAddRowStruct(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "ObjectRecordBase": { var m = new NFMsg.ObjectRecordBase(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "ObjectRecordList": { var m = new NFMsg.ObjectRecordList(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "ObjectRecordInt": { var m = new NFMsg.ObjectRecordInt(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "ObjectRecordFloat": { var m = new NFMsg.ObjectRecordFloat(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "ObjectRecordString": { var m = new NFMsg.ObjectRecordString(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "ObjectRecordObject": { var m = new NFMsg.ObjectRecordObject(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "ObjectRecordVector2": { var m = new NFMsg.ObjectRecordVector2(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "ObjectRecordVector3": { var m = new NFMsg.ObjectRecordVector3(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "ObjectRecordSwap": { var m = new NFMsg.ObjectRecordSwap(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "ObjectRecordAddRow": { var m = new NFMsg.ObjectRecordAddRow(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "ObjectRecordRemove": { var m = new NFMsg.ObjectRecordRemove(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "ServerInfoExt": { var m = new NFMsg.ServerInfoExt(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "ServerInfoReport": { var m = new NFMsg.ServerInfoReport(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "ServerInfoReportList": { var m = new NFMsg.ServerInfoReportList(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "AckEventResult": { var m = new NFMsg.AckEventResult(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "ReqAccountLogin": { var m = new NFMsg.ReqAccountLogin(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "ServerInfo": { var m = new NFMsg.ServerInfo(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "ReqServerList": { var m = new NFMsg.ReqServerList(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "AckServerList": { var m = new NFMsg.AckServerList(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "ReqConnectWorld": { var m = new NFMsg.ReqConnectWorld(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "AckConnectWorldResult": { var m = new NFMsg.AckConnectWorldResult(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "ReqSelectServer": { var m = new NFMsg.ReqSelectServer(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "ReqRoleList": { var m = new NFMsg.ReqRoleList(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "RoleLiteInfo": { var m = new NFMsg.RoleLiteInfo(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "AckRoleLiteInfoList": { var m = new NFMsg.AckRoleLiteInfoList(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "ReqCreateRole": { var m = new NFMsg.ReqCreateRole(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "ReqDeleteRole": { var m = new NFMsg.ReqDeleteRole(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "ServerHeartBeat": { var m = new NFMsg.ServerHeartBeat(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "BatchPropertySync": { var m = new NFMsg.BatchPropertySync(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "BatchPropertyColumns": { var m = new NFMsg.BatchPropertyColumns(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "InterestPosSync": { var m = new NFMsg.InterestPosSync(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "ReqSwitchServer": { var m = new NFMsg.ReqSwitchServer(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "AckSwitchServer": { var m = new NFMsg.AckSwitchServer(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "SwitchServerData": { var m = new NFMsg.SwitchServerData(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "ReqSetFightHero": { var m = new NFMsg.ReqSetFightHero(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "RoleOnlineNotify": { var m = new NFMsg.RoleOnlineNotify(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "RoleOfflineNotify": { var m = new NFMsg.RoleOfflineNotify(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "SwitchNotice": { var m = new NFMsg.SwitchNotice(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "SessionBindNotify": { var m = new NFMsg.SessionBindNotify(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "SwitchRefused": { var m = new NFMsg.SwitchRefused(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "ReqEnterGameServer": { var m = new NFMsg.ReqEnterGameServer(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "PlayerEntryInfo": { var m = new NFMsg.PlayerEntryInfo(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "AckPlayerEntryList": { var m = new NFMsg.AckPlayerEntryList(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "AckPlayerLeaveList": { var m = new NFMsg.AckPlayerLeaveList(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "ReqAckPlayerMove": { var m = new NFMsg.ReqAckPlayerMove(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "ChatContainer": { var m = new NFMsg.ChatContainer(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "ReqAckPlayerChat": { var m = new NFMsg.ReqAckPlayerChat(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "EffectData": { var m = new NFMsg.EffectData(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "ReqAckUseSkill": { var m = new NFMsg.ReqAckUseSkill(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "ReqAckSwapScene": { var m = new NFMsg.ReqAckSwapScene(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "ItemStruct": { var m = new NFMsg.ItemStruct(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "ReqAckUseItem": { var m = new NFMsg.ReqAckUseItem(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "ReqWearEquip": { var m = new NFMsg.ReqWearEquip(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "TakeOffEquip": { var m = new NFMsg.TakeOffEquip(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "ReqAcceptTask": { var m = new NFMsg.ReqAcceptTask(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "ReqCompeleteTask": { var m = new NFMsg.ReqCompeleteTask(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "TeammemberInfo": { var m = new NFMsg.TeammemberInfo(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "TeamInfo": { var m = new NFMsg.TeamInfo(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "ReqAckCreateTeam": { var m = new NFMsg.ReqAckCreateTeam(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "ReqAckJoinTeam": { var m = new NFMsg.ReqAckJoinTeam(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "ReqAckLeaveTeam": { var m = new NFMsg.ReqAckLeaveTeam(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "ReqAckOprTeamMember": { var m = new NFMsg.ReqAckOprTeamMember(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "ReqAckCreateGuild": { var m = new NFMsg.ReqAckCreateGuild(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "ReqAckJoinGuild": { var m = new NFMsg.ReqAckJoinGuild(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "ReqAckLeaveGuild": { var m = new NFMsg.ReqAckLeaveGuild(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "ReqSearchGuild": { var m = new NFMsg.ReqSearchGuild(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "ReqCommand": { var m = new NFMsg.ReqCommand(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "PVPRoomInfo": { var m = new NFMsg.PVPRoomInfo(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "ReqPVPApplyMatch": { var m = new NFMsg.ReqPVPApplyMatch(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "AckPVPApplyMatch": { var m = new NFMsg.AckPVPApplyMatch(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "ReqCreatePVPEctype": { var m = new NFMsg.ReqCreatePVPEctype(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "AckCreatePVPEctype": { var m = new NFMsg.AckCreatePVPEctype(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "SearchGuildObject": { var m = new NFMsg.SearchGuildObject(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "AckSearchGuild": { var m = new NFMsg.AckSearchGuild(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "PackMysqlParam": { var m = new NFMsg.PackMysqlParam(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "PackMysqlServerInfo": { var m = new NFMsg.PackMysqlServerInfo(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "PackSURLParam": { var m = new NFMsg.PackSURLParam(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "ReqAckBuyObjectFormShop": { var m = new NFMsg.ReqAckBuyObjectFormShop(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "ReqAckMoveBuildObject": { var m = new NFMsg.ReqAckMoveBuildObject(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "ReqUpBuildLv": { var m = new NFMsg.ReqUpBuildLv(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "ReqCreateItem": { var m = new NFMsg.ReqCreateItem(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "ReqBuildOperate": { var m = new NFMsg.ReqBuildOperate(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "FSVector3": { var m = new NFMsg.FSVector3(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "Suwayyah": { var m = new NFMsg.Suwayyah(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "SuwayyahEvents": { var m = new NFMsg.SuwayyahEvents(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "TacheBomp": { var m = new NFMsg.TacheBomp(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "Bullet": { var m = new NFMsg.Bullet(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "BulletEvents": { var m = new NFMsg.BulletEvents(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "Move": { var m = new NFMsg.Move(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "AnimatorMoves": { var m = new NFMsg.AnimatorMoves(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "Camera": { var m = new NFMsg.Camera(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "CameraControlEvents": { var m = new NFMsg.CameraControlEvents(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "Particle": { var m = new NFMsg.Particle(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "ParticleEvents": { var m = new NFMsg.ParticleEvents(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "Enable": { var m = new NFMsg.Enable(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "EnableEvents": { var m = new NFMsg.EnableEvents(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "Trail": { var m = new NFMsg.Trail(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "TrailEvents": { var m = new NFMsg.TrailEvents(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "Audio": { var m = new NFMsg.Audio(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "AudioEvents": { var m = new NFMsg.AudioEvents(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "Speed": { var m = new NFMsg.Speed(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "GlobalSpeeds": { var m = new NFMsg.GlobalSpeeds(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "Fly": { var m = new NFMsg.Fly(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            case "AnimatorFlys": { var m = new NFMsg.AnimatorFlys(); if (!m.Decode(raw, 0, raw.Length)) return null; return m.Encode(); }
            default: return null;
        }
    }

    public static int Main(string[] args)
    {
        int bad = 0, n = 0;
        foreach (var line in File.ReadAllLines(args[0]))
        {
            if (line.Length == 0 || line[0] == '#') continue;
            var parts = line.Split('\t');
            var raw = new byte[parts[1].Length / 2];
            for (int i = 0; i < raw.Length; i++)
                raw[i] = Convert.ToByte(parts[1].Substring(2 * i, 2), 16);
            var back = Roundtrip(parts[0], raw);
            n++;
            bool ok = back != null && back.Length == raw.Length;
            if (ok) for (int i = 0; i < raw.Length; i++)
                if (back[i] != raw[i]) { ok = false; break; }
            if (!ok) { bad++; Console.WriteLine("FAIL " + parts[0]); }
        }
        Console.WriteLine(n + " vectors, " + bad + " failures");
        return bad == 0 && n > 0 ? 0 : 1;
    }
}
